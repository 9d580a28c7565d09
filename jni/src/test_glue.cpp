/* Drives the JNIEXPORT layer (jni_glue.cpp) end-to-end over the fake
 * JNIEnv — the role of the reference's JUnit suites without a JVM.
 * Scenario slices ported from:
 *   CastStringsTest.java  — toInteger happy path + ansi CastException
 *   RmmSparkTest.java     — adaptor lifecycle, injected RetryOOM code,
 *                           retry metric, blocked-callback wiring
 * plus handle-lifecycle hardening: double release, bad handle, invoke
 * error mapping.
 *
 * Run by ci/premerge.sh:  jni/test_glue  (needs libsrj_bridge deps and
 * libtpu_resource_adaptor.so; set SRJ_ADAPTOR_LIB).
 */
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#include "fake_jni.h"
#include "jni_stub.h"

extern "C" {
/* the JNIEXPORT surface under test (jni_glue.cpp) */
jint Java_com_nvidia_spark_rapids_jni_NativeDepsLoader_initBridge(
    JNIEnv*, jclass, jstring);
jstring Java_com_nvidia_spark_rapids_jni_NativeDepsLoader_lastError(
    JNIEnv*, jclass);
jlong Java_com_nvidia_spark_rapids_jni_Bridge_columnFromHost(
    JNIEnv*, jclass, jstring, jlong, jbyteArray, jbyteArray, jint, jint);
jlong Java_com_nvidia_spark_rapids_jni_Bridge_stringColumnFromHost(
    JNIEnv*, jclass, jbyteArray, jintArray, jbyteArray, jlong);
jobject Java_com_nvidia_spark_rapids_jni_Bridge_columnToHost(JNIEnv*, jclass,
                                                             jlong);
jlong Java_com_nvidia_spark_rapids_jni_Bridge_numRows(JNIEnv*, jclass, jlong);
void Java_com_nvidia_spark_rapids_jni_Bridge_release(JNIEnv*, jclass, jlong);
jlongArray Java_com_nvidia_spark_rapids_jni_Bridge_invoke(
    JNIEnv*, jclass, jstring, jstring, jlongArray);
jlong Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_create(
    JNIEnv*, jclass, jlong, jstring);
void Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_destroy(
    JNIEnv*, jclass, jlong);
void Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_startDedicatedTaskThread(
    JNIEnv*, jclass, jlong, jlong, jlong);
void Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_removeThreadAssociation(
    JNIEnv*, jclass, jlong, jlong, jlong);
void Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_taskDone(
    JNIEnv*, jclass, jlong, jlong);
jint Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_allocate(
    JNIEnv*, jclass, jlong, jlong, jlong);
void Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_deallocate(
    JNIEnv*, jclass, jlong, jlong, jlong);
jint Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_getStateOf(
    JNIEnv*, jclass, jlong, jlong);
jint Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_checkAndBreakDeadlocks(
    JNIEnv*, jclass, jlong);
void Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_forceRetryOOM(
    JNIEnv*, jclass, jlong, jlong, jint, jint);
jlong Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_getAndResetMetric(
    JNIEnv*, jclass, jlong, jlong, jint);
jlong Java_com_nvidia_spark_rapids_jni_SparkResourceAdaptor_totalAllocated(
    JNIEnv*, jclass, jlong);
}

#define GLUE(name) Java_com_nvidia_spark_rapids_jni_##name

static int g_failures = 0;

#define CHECK(cond, what)                                      \
  do {                                                         \
    if (!(cond)) {                                             \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__,       \
                   __LINE__, what);                            \
      g_failures++;                                            \
    }                                                          \
  } while (0)

static jlong make_string_column(JNIEnv* env,
                                const std::vector<std::string>& vals,
                                const std::vector<bool>& valid) {
  std::string chars;
  std::vector<jint> offs{0};
  std::vector<jbyte> vbytes;
  for (size_t i = 0; i < vals.size(); i++) {
    chars += vals[i];
    offs.push_back(static_cast<jint>(chars.size()));
    vbytes.push_back(valid[i] ? 1 : 0);
  }
  return GLUE(Bridge_stringColumnFromHost)(
      env, nullptr, fakejni::make_bytes(chars.data(), chars.size()),
      fakejni::make_ints(offs.data(), offs.size()),
      fakejni::make_bytes(vbytes.data(), vbytes.size()),
      static_cast<jlong>(vals.size()));
}

static void test_cast_strings(JNIEnv* env) {
  /* CastStringsTest.java happy path: "123", " 456 ", "abc", null */
  std::printf("  cast: building column\n");
  jlong col = make_string_column(env, {"123", " 456 ", "abc", ""},
                                 {true, true, true, false});
  std::printf("  cast: column=%lld\n", (long long)col);
  CHECK(col != 0, "string column handle");
  CHECK(GLUE(Bridge_numRows)(env, nullptr, col) == 4, "numRows");
  std::printf("  cast: numRows ok\n");

  jlong in[] = {col};
  jlongArray out = GLUE(Bridge_invoke)(
      env, nullptr, fakejni::make_string("CastStrings.toInteger"),
      fakejni::make_string("{\"ansi\": false, \"strip\": true, "
                           "\"kind\": \"int32\"}"),
      fakejni::make_longs(in, 1));
  CHECK(out != nullptr && !fakejni::exception_pending(),
        "toInteger non-ansi should succeed");
  auto handles = fakejni::get_longs(out);
  CHECK(handles.size() == 1, "one result handle");

  jobject host = GLUE(Bridge_columnToHost)(env, nullptr, handles[0]);
  CHECK(host != nullptr, "columnToHost");
  CHECK(fakejni::get_long_field(host, "rows") == 4, "host rows");
  auto data = fakejni::get_bytes(fakejni::get_obj_field(host, "data"));
  auto vals = reinterpret_cast<const int32_t*>(data.data());
  CHECK(vals[0] == 123 && vals[1] == 456, "cast values 123/456");
  auto vb = fakejni::get_bytes(fakejni::get_obj_field(host, "validity"));
  CHECK(vb[0] == 1 && vb[1] == 1 && vb[2] == 0 && vb[3] == 0,
        "validity: abc and null rows are null");

  /* ansi mode: "abc" must throw CastException through the glue */
  fakejni::reset();
  jlongArray out2 = GLUE(Bridge_invoke)(
      env, nullptr, fakejni::make_string("CastStrings.toInteger"),
      fakejni::make_string("{\"ansi\": true, \"strip\": true, "
                           "\"kind\": \"int32\"}"),
      fakejni::make_longs(in, 1));
  CHECK(out2 == nullptr, "ansi invoke returns null");
  CHECK(fakejni::exception_pending(), "ansi invoke throws");
  CHECK(fakejni::thrown_class() ==
            "com/nvidia/spark/rapids/jni/CastException",
        "exception class is CastException");
  fakejni::reset();

  /* handle lifecycle: release result + input; double release is a no-op */
  GLUE(Bridge_release)(env, nullptr, handles[0]);
  GLUE(Bridge_release)(env, nullptr, handles[0]);
  GLUE(Bridge_release)(env, nullptr, col);
  GLUE(Bridge_release)(env, nullptr, col);
  /* operating on a released handle must error, not crash */
  CHECK(GLUE(Bridge_numRows)(env, nullptr, col) == -1 ||
            fakejni::exception_pending(),
        "numRows on released handle errors");
  fakejni::reset();

  /* unknown op maps to RuntimeException */
  jlongArray out3 = GLUE(Bridge_invoke)(
      env, nullptr, fakejni::make_string("NoSuch.op"),
      fakejni::make_string("{}"), fakejni::make_longs(in, 0));
  CHECK(out3 == nullptr && fakejni::exception_pending(),
        "unknown op throws");
  CHECK(fakejni::thrown_class() == "java/lang/RuntimeException",
        "unknown op is RuntimeException");
  fakejni::reset();
  std::printf("cast-strings scenarios OK\n");
}

static void test_hash_roundtrip(JNIEnv* env) {
  /* Hash.murmurHash32 over int64 column (HashTest.java slice) */
  int64_t vals[] = {42, -1, 0};
  jlong col = GLUE(Bridge_columnFromHost)(
      env, nullptr, fakejni::make_string("int64"), 3,
      fakejni::make_bytes(vals, sizeof(vals)), nullptr, 0, 0);
  CHECK(col != 0, "int64 column");
  jlong in[] = {col};
  jlongArray out = GLUE(Bridge_invoke)(
      env, nullptr, fakejni::make_string("Hash.murmurHash32"),
      fakejni::make_string("{\"seed\": 42}"), fakejni::make_longs(in, 1));
  CHECK(out != nullptr && !fakejni::exception_pending(), "murmur invoke");
  auto handles = fakejni::get_longs(out);
  jobject host = GLUE(Bridge_columnToHost)(env, nullptr, handles[0]);
  auto data = fakejni::get_bytes(fakejni::get_obj_field(host, "data"));
  CHECK(data.size() == 3 * 4, "3 int32 hashes");
  GLUE(Bridge_release)(env, nullptr, handles[0]);
  GLUE(Bridge_release)(env, nullptr, col);
  std::printf("hash scenario OK\n");
}

static bool blocked_hook(long) { return false; }

static void test_rmm_spark(JNIEnv* env) {
  /* RmmSparkTest.java slice: lifecycle + injected RetryOOM + metrics */
  fakejni::set_blocked_hook(blocked_hook);
  jlong h = GLUE(SparkResourceAdaptor_create)(env, nullptr, 1 << 20,
                                              nullptr);
  CHECK(h != 0 && !fakejni::exception_pending(), "adaptor create");
  jlong tid = 7001, task = 42;
  GLUE(SparkResourceAdaptor_startDedicatedTaskThread)(env, nullptr, h, tid,
                                                      task);
  CHECK(GLUE(SparkResourceAdaptor_getStateOf)(env, nullptr, h, tid) == 1,
        "registered thread RUNNING");

  CHECK(GLUE(SparkResourceAdaptor_allocate)(env, nullptr, h, tid, 1024) == 0,
        "allocate OK code");
  CHECK(GLUE(SparkResourceAdaptor_totalAllocated)(env, nullptr, h) == 1024,
        "totalAllocated tracks");

  /* injected RetryOOM surfaces as the RETRY code (1) like the Java side
   * expects (RmmSparkTest.testRetryOOM) */
  GLUE(SparkResourceAdaptor_forceRetryOOM)(env, nullptr, h, tid, 1, 0);
  CHECK(GLUE(SparkResourceAdaptor_allocate)(env, nullptr, h, tid, 16) == 1,
        "injected RetryOOM code");
  CHECK(GLUE(SparkResourceAdaptor_getAndResetMetric)(env, nullptr, h, task,
                                                     0) == 1,
        "numRetry metric");

  /* the blocked-thread callback reaches the fake JVM during deadlock
   * scans (is_thread_blocked_cb -> CallStaticBooleanMethod) */
  long before = fakejni::blocked_calls();
  GLUE(SparkResourceAdaptor_checkAndBreakDeadlocks)(env, nullptr, h);
  CHECK(fakejni::blocked_calls() > before,
        "ThreadStateRegistry callback crossed the (fake) JNI boundary");

  GLUE(SparkResourceAdaptor_deallocate)(env, nullptr, h, tid, 1024);
  GLUE(SparkResourceAdaptor_taskDone)(env, nullptr, h, task);
  GLUE(SparkResourceAdaptor_removeThreadAssociation)(env, nullptr, h, tid,
                                                     -1);
  GLUE(SparkResourceAdaptor_destroy)(env, nullptr, h);
  std::printf("rmm-spark scenarios OK\n");
}

#include <execinfo.h>
#include <csignal>

static void segv_handler(int sig) {
  void* frames[32];
  int n = backtrace(frames, 32);
  std::fprintf(stderr, "signal %d; backtrace:\n", sig);
  backtrace_symbols_fd(frames, n, 2);
  _exit(139);
}

#include <atomic>
#include <chrono>
#include <thread>

static void test_rmm_blocking(JNIEnv* env) {
  /* RmmSparkTest.testBasicBlocking port: a second task's allocate parks
   * in BLOCKED until the first frees; codes and states observed through
   * the glue exactly as the Java side would. */
  jlong h = GLUE(SparkResourceAdaptor_create)(env, nullptr, 1 << 20,
                                              nullptr);
  CHECK(h != 0, "adaptor create (blocking)");
  jlong t1 = 8101, t2 = 8102;
  GLUE(SparkResourceAdaptor_startDedicatedTaskThread)(env, nullptr, h, t1,
                                                      1);
  GLUE(SparkResourceAdaptor_startDedicatedTaskThread)(env, nullptr, h, t2,
                                                      2);
  CHECK(GLUE(SparkResourceAdaptor_allocate)(env, nullptr, h, t1,
                                            900 << 10) == 0,
        "t1 allocate ok");

  std::atomic<int> t2_code{-99};
  std::thread blocked([&] {
    t2_code = GLUE(SparkResourceAdaptor_allocate)(env, nullptr, h, t2,
                                                  900 << 10);
  });
  /* poll for BLOCKED(4) like RmmSparkTest.pollForState */
  int state = 0;
  for (int i = 0; i < 200; i++) {
    state = GLUE(SparkResourceAdaptor_getStateOf)(env, nullptr, h, t2);
    if (state == 4) break;
    std::this_thread::sleep_for(std::chrono::milliseconds(5));
  }
  CHECK(state == 4, "t2 reaches BLOCKED");
  GLUE(SparkResourceAdaptor_deallocate)(env, nullptr, h, t1, 900 << 10);
  blocked.join();
  CHECK(t2_code == 0, "t2 allocate completed after the free");
  CHECK(GLUE(SparkResourceAdaptor_getAndResetMetric)(env, nullptr, h, 2,
                                                     2) > 0,
        "t2 block time metric");
  GLUE(SparkResourceAdaptor_deallocate)(env, nullptr, h, t2, 900 << 10);
  GLUE(SparkResourceAdaptor_taskDone)(env, nullptr, h, 1);
  GLUE(SparkResourceAdaptor_taskDone)(env, nullptr, h, 2);
  GLUE(SparkResourceAdaptor_destroy)(env, nullptr, h);
  std::printf("rmm-blocking scenario OK\n");
}

int main() {
  std::signal(SIGSEGV, segv_handler);
  std::signal(SIGABRT, segv_handler);
  setvbuf(stdout, nullptr, _IONBF, 0);
  setvbuf(stderr, nullptr, _IONBF, 0);
  /* the embedded interpreter runs on the CPU: the package __init__
   * honors SRJ_FORCE_CPU */
  setenv("SRJ_FORCE_CPU", "1", 1);
  JNIEnv* env = fakejni::env();
  std::printf("stage: init\n");

  const char* root = std::getenv("SRJ_PY_ROOT");
  jint rc = GLUE(NativeDepsLoader_initBridge)(
      env, nullptr, fakejni::make_string(root != nullptr ? root : "."));
  if (rc != 0) {
    jstring err = GLUE(NativeDepsLoader_lastError)(env, nullptr);
    std::fprintf(stderr, "initBridge failed: %s\n",
                 fakejni::get_string(err).c_str());
    return 2;
  }

  /* pure-host op first: isolates embedded-jax-compute crashes */
  std::printf("stage: tz\n");
  jlongArray tzout = GLUE(Bridge_invoke)(
      env, nullptr, fakejni::make_string("GpuTimeZoneDB.isSupportedTimeZone"),
      fakejni::make_string("{\"zone\": \"America/Los_Angeles\"}"),
      fakejni::make_longs(nullptr, 0));
  std::printf("stage: tz done (%p, pending=%d)\n", (void*)tzout,
              (int)fakejni::exception_pending());
  fakejni::reset();

  std::printf("stage: cast\n");
  test_cast_strings(env);
  std::printf("stage: hash\n");
  test_hash_roundtrip(env);
  std::printf("stage: rmm\n");
  test_rmm_spark(env);
  std::printf("stage: rmm-blocking\n");
  test_rmm_blocking(env);

  if (g_failures != 0) {
    std::fprintf(stderr, "%d glue checks FAILED\n", g_failures);
    return 1;
  }
  std::printf("ALL GLUE SCENARIOS OK\n");
  return 0;
}
